"""The unit kind ``train``: one whole ``Workflow.train()`` a unit.

A traffic file names its unit kind under ``unit``; ``chipbench.run`` finds
this module by that name and asks it for everything that depends on what a
unit is: the set-up (warm-up unit, staged inputs), the unit itself, the
end-to-end metric, and what is collected and compared afterwards. Each unit
trains on its own seeded row permutation of the run's table, applied on the
host before the window opens.
"""

from __future__ import annotations

import importlib
import time

from chipbench import data, pipeline, window


def train_unit(frame, pcfg: dict):
    """One whole ``Workflow.train()``, ending in results on the host."""
    wf, handles = pipeline.build_workflow(frame, pcfg)
    model = wf.train()
    summary = model.selector_summary()
    _ = summary.holdout_evaluation       # host floats: the train has ended
    return model, handles, summary


class Prepared:
    """Set-up of a run of train units: the table from the seed, one warm-up
    train of the same shapes on a table of another seed stream, and every
    unit's permuted frame staged on the host."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, rng, log):
        self.config, self.pcfg = config, config["pipeline"]
        self.n_rows = int(config["rows"])
        self.compare = importlib.import_module(config["comparison"])
        log(f"making {self.n_rows} rows from seed {seed}")
        self.table = data.make_table(config["dataset"], self.n_rows, seed)
        warm = data.make_table(config["dataset"], self.n_rows, seed, stream=1)
        t0 = time.perf_counter()
        self.run_unit(pipeline.to_frame(warm))
        warm_s = time.perf_counter() - t0
        log(f"warm-up train {warm_s:.1f}s")
        n_units = window.units_to_stage(seconds, warm_s)
        self.perms = [rng.permutation(self.n_rows) for _ in range(n_units)]
        self.staged = [pipeline.to_frame(self.table.take(p))
                       for p in self.perms]

    def run_unit(self, frame):
        # looked up at call time: the tests break the timed path here
        return train_unit(frame, self.pcfg)

    def end_to_end(self, walls: list, window_s: float) -> dict:
        return {"automl_wall_s": window_s / len(walls)}

    def rows_worked(self, n_units: int) -> int:
        return self.n_rows * n_units

    def attempted(self, n_units: int) -> int:
        return n_units

    def collect(self, results: list, pick: int, rng) -> dict:
        """What unit ``pick`` produced, as host numbers."""
        model, handles, summary = results[pick]
        self.unit_table = self.table.take(self.perms[pick])
        return self.compare.collect(model, handles, summary,
                                    self.staged[pick], self.pcfg, rng)

    def free(self) -> None:
        """Drop the program's state before the reference runs."""
        self.staged = self.table = self.perms = None

    def check(self, produced: dict) -> dict:
        return self.compare.check(produced, self.unit_table, self.config)

    def shapes(self, produced: dict) -> dict:
        return self.compare.shapes_of(produced, self.config, self.n_rows)

    def window_flops(self, shapes: dict, n_units: int) -> dict:
        return self.compare.window_flops(self.config, shapes, n_units)


def prepare(config, traffic, seed, seconds, rng, log) -> Prepared:
    return Prepared(config, traffic, seed, seconds, rng, log)
