"""Read the CONTROL, the planted FAULTS and the PROGRAM at a cell's own size.

    python3 -m chipbench.control --workload <name> --seeds 11,12,13
    python3 -m chipbench.control --workload <name> --seeds 11 --fault half
    python3 -m chipbench.control --workload <name> --seeds 11,12 --program

The control is the plain reference one precision step down (bfloat16 for
the float32 linear algebra and the feature matrix, float8 gradients for the
bfloat16 histogram operands), put in the program's place and compared with
the same code and limits as a run. It has to come out NOT correct, and so
has each fault. The benchmark's own runs never call this; PERF.md records
its readings, and ``tests/test_faults.py`` keeps it as a test at a size a
test run can hold. This is the one entry point of the control. With
``--program`` it reads the PROGRAM instead: one whole timed unit a seed, no
warm-up and no window, in one process, for the lower readings that the
limits are set from where a full run a seed costs too much set-up; that
has to come out correct.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time

import numpy as np

from chipbench import run as _run            # fixes the cache's place first

FAULTS = ("half", "stale", "winner")


def _unit(config: dict, compare, seed: int) -> tuple:
    """``(table, produced-to-be)`` of one train unit drawn as a run draws
    its units: the seed's table under a seeded row permutation, and the
    rows whose vectors and scores are compared."""
    from chipbench import data
    n_rows = int(config["rows"])
    rng = np.random.default_rng(np.random.SeedSequence([seed, 99]))
    table = data.make_table(config["dataset"], n_rows, seed)
    table = table.take(rng.permutation(n_rows))
    idx, hidx = compare.sample_rows(n_rows, config["pipeline"], rng)
    return table, {"n_rows": n_rows, "sample_idx": idx, "holdout_rows": hidx}


def numbers_of(config: dict, seed: int, fault: str | None) -> dict:
    """The reference put in the program's place and read against the honest
    reference of the unit. With no ``fault`` it runs one precision step down
    (the control). ``half`` trains on the first half of the unit's rows;
    ``stale`` returns the model of another table (a step that left its state
    as the warm-up made it); ``winner`` names the runner-up as the winner
    (an answer altered where it is produced)."""
    from chipbench import data, reference
    compare = importlib.import_module(config["comparison"])
    pcfg, n_rows = config["pipeline"], int(config["rows"])
    table, produced = _unit(config, compare, seed)
    if fault == "half":
        other = table.take(np.arange(n_rows // 2))
    elif fault == "stale":
        other = data.make_table(config["dataset"], n_rows, seed, stream=1)
    else:
        other = table
    bad = reference.reference_train(other, pcfg, sweep=True,
                                    lowp=fault is None)
    ranked = sorted(bad.cv, key=bad.cv.get)
    produced["winner"] = ranked[-2 if fault == "winner" else -1]
    produced = compare.as_program(bad, table, produced, pcfg,
                                  lowp=fault is None)
    del bad
    return compare.check(produced, table, config)


def program_numbers(config: dict, seed: int) -> dict:
    """One whole unit of the program itself on the seed's unit table, read
    back and compared as a run does once its window has closed."""
    import gc

    import jax

    from chipbench import pipeline
    from chipbench.units import train as kind
    compare = importlib.import_module(config["comparison"])
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    table, _ = _unit(config, compare, seed)
    frame = pipeline.to_frame(table)
    model, handles, summary = kind.train_unit(frame, config["pipeline"])
    produced = compare.collect(model, handles, summary, frame,
                               config["pipeline"], rng)
    del model, handles, summary, frame
    gc.collect()
    jax.clear_caches()
    return compare.check(produced, table, config)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", choices=FAULTS, default=None,
                    help="plant this fault instead of the lower precision")
    ap.add_argument("--program", action="store_true",
                    help="read the program itself, one unit a seed: has to "
                         "come out correct")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    _, cell, config, _ = _run.load_cell(args.workload, args.rows)
    if not args.allow_cpu:
        _run.require_tpu(int(cell["chips"]))
    elif args.program:
        _run.rehearse_off_chip(config)
    _run.enable_cache()
    limits = config["limits"]
    all_failed = all_passed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if args.program:
            numbers = program_numbers(config, seed)
        else:
            numbers = numbers_of(config, seed, args.fault)
        over = sorted(k for k, v in numbers.items()
                      if k in limits and not v <= limits[k])
        over += sorted(f"no reading: {k}" for k in set(limits) - set(numbers))
        all_failed &= bool(over)
        all_passed &= not over
        planted = args.fault or "lower precision"
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "planted": "nothing" if args.program else planted,
                          "control_correct": not over, "over": over,
                          "numbers": numbers,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0 if (all_passed if args.program else all_failed) else 1


if __name__ == "__main__":
    sys.exit(main())
