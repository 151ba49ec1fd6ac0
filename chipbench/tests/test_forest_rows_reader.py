"""The reader of ``forest_rows_carried_pct`` (PR 34), on hand-made
``RunFacts``: a recorded run, a run whose every tree moved all its rows, a
program older than the counters and a run that grew no forest.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""

from __future__ import annotations

import pytest

from chipbench.layer_metrics import forest_rows_carried_pct
from chipbench.run import RunFacts


@pytest.mark.parametrize("counters,want", [
    # two trains of a window: the sweep's forest programs and the refit
    ({"forestRowsTotal": 2 * 54_905_739, "forestRowsCarried": 2 * 36_153_600,
      "sweepHostSyncs": 2}, 100 * 36_153_600 / 54_905_739),
    # every tree moved all its rows (a small table, the scatter engine)
    ({"forestRowsTotal": 9_000, "forestRowsCarried": 9_000}, 100.0),
    # a program older than the counters, and a run that grew no forest
    ({"sweepHostSyncs": 1, "sweepLoopFallbacks": 0}, None),
    ({}, None),
])
def test_forest_rows_carried_pct(counters, want):
    run = RunFacts(cell={}, config={}, traffic={}, units=2,
                   counters=counters)
    got = forest_rows_carried_pct.read(run)
    assert got is None if want is None else got == pytest.approx(want)
