"""The raw host table a configuration's ``dataset`` block describes.

A configuration names its generator in ``configs/<name>.json`` under
``dataset.generator``; the generator is the module
``chipbench/generators/<generator>.py`` with a function ``make(n, seed,
spec, stream) -> Table``. Nothing here knows a generator or a configuration
by name: a later PR adds a generator by adding its file.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Table:
    """A raw host table: float columns, categorical columns, label.

    ``cats`` holds object arrays of strings with ``None`` for nulls;
    ``cat_codes`` the integer codes they were made from (-1 for null), the
    generator's own knowledge, which a plain reference may use in place of
    re-deriving the codes from the strings."""
    nums: dict
    cats: dict = field(default_factory=dict)
    cat_codes: dict = field(default_factory=dict)
    cat_cards: dict = field(default_factory=dict)
    label: np.ndarray = None

    @property
    def n_rows(self) -> int:
        return int(self.label.shape[0])

    def take(self, idx: np.ndarray) -> "Table":
        return Table(
            nums={k: v[idx] for k, v in self.nums.items()},
            cats={k: v[idx] for k, v in self.cats.items()},
            cat_codes={k: v[idx] for k, v in self.cat_codes.items()},
            cat_cards=dict(self.cat_cards),
            label=self.label[idx])


def seeded(seed: int, stream: int) -> np.random.Generator:
    """A generator for ``(seed, stream)``; ``seed`` is any whole number up to
    a little over 2**31, which ``SeedSequence`` takes as it is."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def make_table(dataset: dict, n_rows: int, seed: int, stream: int = 0
               ) -> Table:
    gen = importlib.import_module(
        f"chipbench.generators.{dataset['generator']}")
    return gen.make(int(n_rows), int(seed), dataset, stream)
