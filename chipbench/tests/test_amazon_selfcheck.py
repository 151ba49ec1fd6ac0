"""The ``amazon_text_train`` cell's self-check, off the chip.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_amazon_selfcheck.py

A sound run at a size a test can hold is correct; the control (the
reference with bfloat16 products in its trainers, over the exact feature
matrix, in the program's place: it fails the Newton points' fold metrics),
half of the rows and another table's model are not; the runner-up named winner is told
wherever it lies further behind than the limit on ``winner_regret`` (the
four pure-L2 points are all Newton points and finish within 1e-3 of one
another: naming the second of them costs its regret and nothing else); the
new per-layer readers return a value on a recorded ``RunFacts`` and
``None`` on an empty one, as they do on the parent commit's program. The
size: 40,000 rows, where 200 Adam steps have converged far enough that a
pure-L2 point wins, as at the cell's size. Slow (one to three minutes a
case on the CPU).
"""

from __future__ import annotations

import importlib
import json

import pytest

from chipbench import control_amazon, run
from chipbench.run import RunFacts

SEED, ROWS = "11", 40_000
ARGS = ["--workload", "amazon_text_train", "--allow-cpu", "--rows",
        str(ROWS)]


def test_a_sound_run_is_correct():
    rc = run.main([*ARGS, "--seed", SEED, "--seconds", "1", "--trace", "0"])
    res = run.LAST
    assert rc == 0 and res["correct"], res["compared"]
    assert res["attempted"] == 1 and res["failed"] == 0
    assert res["compared"]["fe_max_abs"]["value"] == 0.0
    assert res["compared"]["fe_hash_fallbacks"]["value"] == 0.0


@pytest.mark.parametrize("fault,over", [
    (None, "cv_gap_lr_l2"), ("half", "holdout_score_gap_linear"),
    ("stale", "holdout_score_gap_linear"), ("winner", "winner_regret")])
def test_control_and_faults_are_not_correct(capsys, fault, over):
    argv = [*ARGS, "--seeds", SEED] + (["--fault", fault] if fault else [])
    rc = control_amazon.main(argv)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    if fault == "winner" and not line["over"]:
        # the runner-up lies inside the limit: no fault it is meant to tell
        config = run.load_cell("amazon_text_train")[2]
        assert line["numbers"]["ref_top2_gap"] <= \
            config["limits"]["winner_regret"]
        return
    assert not line["control_correct"]
    assert over in line["over"], line["over"]


def read(name: str, facts):
    return importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(facts)


NEW = ("fe_tokenize_host_s", "fe_text_python_rows", "fe_text_fill_roofline")


def test_new_readers_are_silent_where_there_is_nothing_to_read():
    empty = RunFacts(cell={}, config={}, traffic={})
    assert [read(n, empty) for n in NEW] == [None] * len(NEW)
    # the parent commit's program: other spans, other counters, no
    # ``_fill_text_entries`` program in the trace
    from types import SimpleNamespace as NS
    older = RunFacts(
        cell={}, config={}, traffic={}, units=2,
        counters={"sweepHostSyncs": 2, "feHashPerRowFallbacks": 4},
        spans=[(0.0, 1.0, "fe.dict_encode"), (1.0, 90.0, "fe.hash")],
        shapes={"n_rows": 1000, "text_blocks": 2, "text_block_width": 514},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace=NS(kernel_seconds=lambda match: 3.0 if match(
            "jit__dense_from_entries/fusion") else 0.0))
    assert [read(n, older) for n in NEW] == [None] * len(NEW)


def test_new_readers_read_a_recorded_run():
    from types import SimpleNamespace as NS
    spans = [(0.0, 0.25, "fe.scan"), (0.25, 0.5, "fe.tokenize"),
             (0.5, 1.5, "fe.tokenize"), (1.5, 1.75, "fe.upload"),
             (10.0, 10.25, "fe.tokenize"), (10.25, 11.5, "fe.tokenize")]
    trace = NS(kernel_seconds=lambda match: 0.5 if match(
        "jit__fill_text_entries/scatter") and match(
        "jit__fill_text_entries_block/fusion") and not match(
        "jit__dense_from_entries/fusion") else 0.0)
    facts = RunFacts(
        cell={}, config={}, traffic={}, units=2, spans=spans,
        counters={"feTextPythonRows": 3672},
        shapes={"n_rows": 360_000, "text_blocks": 2,
                "text_block_width": 514}, trace=trace,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert read("fe_tokenize_host_s", facts) == pytest.approx(1.375)
    assert read("fe_text_python_rows", facts) == 1836.0
    # two trains, two blocks of 360,000 x 514 float32 each, written once
    least = 2 * 2 * 360_000 * 514 * 4.0 / 819e9
    assert read("fe_text_fill_roofline", facts) == pytest.approx(
        100 * least / 0.5)
    assert 0 < read("fe_text_fill_roofline", facts) < 100
