"""The comparison has been shown to fail: the control and the planted faults.

The control (the reference one precision step down, in the program's place)
is read through its one entry point, ``chipbench.control``. Each fault test
skips the harness's look for a chip (``--allow-cpu``) and drives the rest of
a run at a size a test can hold, with the timed path broken underneath, and
sees ``correct`` come out false. Slow (a minute or so each on the CPU): run
with ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests``.
"""

from __future__ import annotations

import json

import numpy as np

from chipbench import control, run
from chipbench.units import train as train_kind

SEED = "2147483777"
ROWS = 40_000       # the least size at which the tree winner of the cell wins
ARGS = ["--seed", SEED, "--seconds", "1", "--trace", "0", "--allow-cpu"]


def drive(workload: str, rows: int = ROWS) -> dict:
    rc = run.main(["--workload", workload, "--rows", str(rows), *ARGS])
    assert rc in (0, 1)
    assert run.LAST["correct"] == (rc == 0)
    return run.LAST


def over(result: dict) -> list:
    return [k for k, c in result["compared"].items()
            if isinstance(c, dict) and not c["value"] <= c["limit"]]


def test_a_sound_run_is_correct():
    res = drive("higgs_train")
    assert res["correct"] and not over(res)
    assert res["attempted"] == 1 and res["failed"] == 0


def test_control_one_precision_step_down_is_not_correct(capsys):
    rc = control.main(["--workload", "higgs_train", "--rows", str(ROWS),
                       "--seeds", SEED, "--allow-cpu"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and not line["control_correct"]
    assert "fe_max_abs" in line["over"]


def test_the_runner_up_named_winner_is_not_correct(capsys):
    rc = control.main(["--workload", "higgs_train", "--rows", str(ROWS),
                       "--seeds", SEED, "--allow-cpu", "--fault", "winner"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and "winner_regret" in line["over"]


def test_a_train_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    real = train_kind.train_unit
    first = []

    def stale(frame, pcfg):
        if not first:
            first.append(real(frame, pcfg))
        return first[0]         # the warm-up's model, whatever the table

    monkeypatch.setattr(train_kind, "train_unit", stale)
    res = drive("higgs_train")
    assert not res["correct"] and over(res)


def test_half_of_the_rows_left_out_is_not_correct(monkeypatch):
    real = train_kind.train_unit

    def half(frame, pcfg):
        return real(frame.take(np.arange(frame.n_rows // 2)), pcfg)

    monkeypatch.setattr(train_kind, "train_unit", half)
    res = drive("higgs_train")
    assert not res["correct"] and over(res)


def test_altered_answers_are_not_correct(monkeypatch):
    """Two of every three scores the refit winner serves, moved where they
    are produced: only the per-row comparison of holdout scores can see it.
    (The median that is compared sees an alteration of more than half of
    the scores; a smaller share shows in the reported mean and tails.)"""
    real = train_kind.train_unit

    def altered(frame, pcfg):
        model, handles, summary = real(frame, pcfg)
        score = model.score

        def moved(fr_, *a, **kw):
            out = score(fr_, *a, **kw)
            for i, doc in enumerate(out[handles["prediction"].name].values):
                if i % 3:
                    for key in ("probability_1", "rawPrediction_1",
                                "rawPrediction_0"):
                        if key in doc:
                            doc[key] = float(doc[key]) + 0.25
            return out

        model.score = moved
        return model, handles, summary

    monkeypatch.setattr(train_kind, "train_unit", altered)
    res = drive("higgs_train")
    assert not res["correct"]
    assert over(res) == ["holdout_score_gap_tree"]
