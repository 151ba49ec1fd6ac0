"""The unit kind ``train_typed``: one whole ``Workflow.train()`` a unit, on a
frame whose columns carry the feature types the configuration states.

The same unit, set-up, metric and comparison as ``chipbench.units.train``
(whose ``Prepared`` this reuses); only how a table becomes a frame differs:
``chipbench.pipeline.to_frame`` types every numeric column Real and every
string Text, where this types each column as the configuration's
``dataset.types`` says (``PickList``, ``DateTime``, ``Integral``, a
``Geolocation`` built from a latitude and a longitude column, ...), so that
``transmogrify`` picks the vectorizer of each type.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from chipbench import data, window
from chipbench.units import train


def to_frame(table, dataset: dict):
    """The raw ``HostFrame`` of a :class:`chipbench.data.Table`, each column
    typed by ``dataset["types"]`` (in that order), no nulls, then the label
    as ``RealNN``."""
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.types import feature_types as ft
    n = table.n_rows
    present = np.ones(n, bool)
    cols = {}
    for name, spec in dataset["types"].items():
        ftype = getattr(ft, spec["type"])
        if ftype.device_kind == "geolocation":
            values = np.stack([table.nums[spec["lat"]],
                               table.nums[spec["lon"]],
                               np.full(n, float(spec["accuracy"]))], axis=1)
            cols[name] = fr.HostColumn(ftype, values, present)
        elif ftype.device_kind in fr.NUMERIC_KINDS:
            cols[name] = fr.HostColumn(
                ftype, np.asarray(table.nums[name], np.float64), present)
        else:
            cols[name] = fr.HostColumn(
                ftype, np.asarray(table.cats[name], dtype=object))
    cols["label"] = fr.HostColumn(
        ft.RealNN, np.asarray(table.label, np.float64), present)
    return fr.HostFrame(cols)


class Prepared(train.Prepared):
    """``train.Prepared`` with typed frames: the table from the seed, one
    warm-up train of the same shapes on a table of another seed stream, and
    every unit's permuted frame staged on the host."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, rng, log):
        self.config, self.pcfg = config, config["pipeline"]
        self.n_rows = int(config["rows"])
        self.compare = importlib.import_module(config["comparison"])
        dataset = config["dataset"]
        log(f"making {self.n_rows} rows from seed {seed}")
        self.table = data.make_table(dataset, self.n_rows, seed)
        warm = data.make_table(dataset, self.n_rows, seed, stream=1)
        t0 = time.perf_counter()
        self.run_unit(to_frame(warm, dataset))
        warm_s = time.perf_counter() - t0
        log(f"warm-up train {warm_s:.1f}s")
        n_units = window.units_to_stage(seconds, warm_s)
        self.perms = [rng.permutation(self.n_rows) for _ in range(n_units)]
        self.staged = [to_frame(self.table.take(p), dataset)
                       for p in self.perms]


def prepare(config, traffic, seed, seconds, rng, log) -> Prepared:
    return Prepared(config, traffic, seed, seconds, rng, log)
