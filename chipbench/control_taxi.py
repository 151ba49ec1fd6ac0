"""The CONTROL, the planted FAULTS and the PROGRAM of the
``nyc_taxi_duration`` configuration, at a cell's own size.

    python3 -m chipbench.control_taxi --workload taxi_duration_train \\
        --seeds 11,12
    python3 -m chipbench.control_taxi --workload taxi_duration_train \\
        --seeds 11 --fault half          (or stale, winner, all)
    python3 -m chipbench.control_taxi --workload taxi_duration_train \\
        --seeds 11 --program

``chipbench.control``'s entry point over ``reference_taxi``, with the same
output lines. The control is that reference one precision step down (the
feature matrix rounded to bfloat16, the Gram's products of bfloat16
columns, float8 tree operands) put in the program's place; ``half`` trains
on half of the unit's rows, ``stale`` returns the model of another table,
``winner`` names the runner-up. Each has to come out NOT correct under the
configuration's limits. ``--fault all`` reads the control and the three
faults of a seed against ONE honest reference of its unit. ``--program``
(one whole unit of the program a seed on a typed frame, no warm-up and no
window) has to come out correct.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time

import numpy as np

from chipbench import control, data
from chipbench import reference_taxi as reference
from chipbench import run as _run

PLANTS = (None,) + control.FAULTS


def numbers_of(config: dict, seed: int, fault: str | None, honest=None
               ) -> dict:
    """The reference with ``fault`` planted (``None``: one precision step
    down) put in the program's place and read against the honest reference
    of the seed's unit."""
    compare = importlib.import_module(config["comparison"])
    n_rows = int(config["rows"])
    table, produced = control._unit(config, compare, seed)
    if fault == "half":
        other = table.take(np.arange(n_rows // 2))
    elif fault == "stale":
        other = data.make_table(config["dataset"], n_rows, seed, stream=1)
    else:
        other = table
    if fault == "winner" and honest is not None:
        bad = honest                    # the honest sweep, another winner
    else:
        bad = reference.reference_train(other, config, sweep=True,
                                        lowp=fault is None)
    ranked = sorted(bad.cv, key=bad.cv.get)          # the least RMSE wins
    produced["winner"] = ranked[1 if fault == "winner" else 0]
    produced = compare.as_program(bad, table, produced, config,
                                  lowp=fault is None)
    del bad
    return compare.check(produced, table, config, ref=honest)


def program_numbers(config: dict, seed: int) -> dict:
    """One whole unit of the program itself on the seed's unit table, typed
    as a run types it, read back and compared as a run does once its window
    has closed."""
    import gc

    import jax

    from chipbench.units import train, train_typed
    compare = importlib.import_module(config["comparison"])
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    table, _ = control._unit(config, compare, seed)
    frame = train_typed.to_frame(table, config["dataset"])
    model, handles, summary = train.train_unit(frame, config["pipeline"])
    produced = compare.collect(model, handles, summary, frame,
                               config["pipeline"], rng)
    del model, handles, summary, frame
    gc.collect()
    jax.clear_caches()
    return compare.check(produced, table, config)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.control_taxi")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", choices=control.FAULTS + ("all",),
                    default=None)
    ap.add_argument("--program", action="store_true")
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
    compare = importlib.import_module(config["comparison"])
    as_expected = True
    for seed in (int(s) for s in args.seeds.split(",")):
        honest = None
        if args.fault == "all":
            table, _ = control._unit(config, compare, seed)
            honest = reference.reference_train(table, config, sweep=True)
            del table
        plants = PLANTS if args.fault == "all" else (args.fault,)
        for plant in ("program",) if args.program else plants:
            t0 = time.perf_counter()
            if args.program:
                numbers = program_numbers(config, seed)
            else:
                numbers = numbers_of(config, seed, plant, honest)
            over = sorted(k for k, v in numbers.items()
                          if k in limits and not v <= limits[k])
            over += sorted(f"no reading: {k}"
                           for k in set(limits) - set(numbers))
            as_expected &= (not over) if args.program else bool(over)
            planted = "nothing" if args.program else (
                plant or "lower precision")
            print(json.dumps({"workload": cell["name"], "seed": seed,
                              "planted": planted,
                              "control_correct": not over, "over": over,
                              "numbers": numbers,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
