"""The measured window: whole units, back to back, ending at a boundary."""

from __future__ import annotations

import time
from typing import Callable, Sequence


def run_window(units: Sequence, run_unit: Callable, seconds: float,
               clock: Callable[[], float] = time.perf_counter):
    """Run ``run_unit(u)`` for ``u`` in ``units`` until the next would not
    fit: a new unit starts only while the elapsed time plus the longest unit
    seen so far still fits ``seconds``; at least one unit always runs, and
    the window ends with the unit that is running. Returns ``(results,
    unit walls, window wall)``: every metric divides by, or sums over, all
    of them."""
    if not units:
        raise ValueError("a window needs at least one staged unit")
    results, walls = [], []
    t_open = clock()
    for u in units:
        t0 = clock()
        results.append(run_unit(u))
        t1 = clock()
        walls.append(t1 - t0)
        if (t1 - t_open) + max(walls) > seconds:
            break
    return results, walls, clock() - t_open


def units_to_stage(seconds: float, warm_unit_s: float, slack: float = 0.75,
                   cap: int = 64) -> int:
    """How many units to stage before the window opens: enough that the
    window cannot run out if every unit ran in ``slack`` of the warm-up
    unit's time, plus one."""
    return max(1, min(cap, int(seconds / max(warm_unit_s * slack, 1e-3)) + 2))
