"""The ``sweep_linear_onepass_lanes`` reader on hand-made ``RunFacts``.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""

from __future__ import annotations

from chipbench.layer_metrics import sweep_linear_onepass_lanes
from chipbench.run import RunFacts


def test_silent_on_a_program_older_than_the_counter():
    assert sweep_linear_onepass_lanes.read(
        RunFacts(cell={}, config={}, traffic={})) is None
    older = RunFacts(cell={}, config={}, traffic={}, units=2,
                     counters={"linearDescentLanes": 74})
    assert sweep_linear_onepass_lanes.read(older) is None


def test_reads_lanes_per_train():
    # two criteo_ctr trains: 8 x 3 + 4 x 3 sweep lanes and a warm refit
    facts = RunFacts(cell={}, config={}, traffic={}, units=2,
                     counters={"linearDescentLanes": 74,
                               "linearOnePassLanes": 74})
    assert sweep_linear_onepass_lanes.read(facts) == 37.0
