"""Click-log-shaped data: 13 count columns, 26 skewed categorical columns.

The shape of the Criteo Display Advertising Challenge table. Categorical
column ``j`` draws a rank from Zipf(``zipf_exponent``) over its published
cardinality; rank ``r`` of column ``j`` is always the same 8-character
hexadecimal string (a fixed bijection of 32-bit integers, no seed), so a
category is the same string in every table. Nulls are drawn per column at
the configuration's rates. Numeric columns are floored log-normal counts,
without nulls. The label is logistic in three numeric columns and in
per-category effects of four categorical columns (fixed like the strings),
with the configuration's intercept (a click rate of about a quarter).
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench.data import Table, seeded

_M32 = np.uint64(0xFFFFFFFF)


def _mix32(x: np.ndarray) -> np.ndarray:
    """A bijection of the 32-bit integers (odd multiplications and xor
    shifts), so distinct ``(column, rank)`` give distinct strings."""
    x = x.astype(np.uint64) & _M32
    x ^= x >> np.uint64(16)
    x = (x * np.uint64(0x7FEB352D)) & _M32
    x ^= x >> np.uint64(15)
    x = (x * np.uint64(0x846CA68B)) & _M32
    x ^= x >> np.uint64(16)
    return x


def category_ids(col: int, ranks: np.ndarray) -> np.ndarray:
    """The 32-bit identity of each ``(column, rank)``: ranks stay under
    2**24 (the largest published cardinality is 10,131,227)."""
    return _mix32((np.uint64(col + 1) << np.uint64(24))
                  | ranks.astype(np.uint64))


def category_effect(col: int, ranks: np.ndarray, scale: float) -> np.ndarray:
    """A fixed effect in ``[-scale, scale]`` per ``(column, rank)``."""
    u = _mix32(category_ids(col, ranks) ^ np.uint64(0x9E3779B9))
    return scale * (u.astype(np.float64) / 2.0 ** 31 - 1.0)


@functools.lru_cache(maxsize=None)
def _zipf_cdf(cardinality: int, exponent: float) -> np.ndarray:
    w = np.arange(1, cardinality + 1, dtype=np.float64) ** -exponent
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def _strings(col: int, ranks: np.ndarray, null: np.ndarray) -> np.ndarray:
    uniq, inv = np.unique(ranks, return_inverse=True)
    names = np.array([format(int(v), "08x")
                      for v in category_ids(col, uniq)], dtype=object)
    out = names[inv]
    out[null] = None
    return out


def make(n: int, seed: int, spec: dict, stream: int = 0) -> Table:
    rng = seeded(seed, stream)
    cards = [int(c) for c in spec["cardinalities"]]
    exponent = float(spec["zipf_exponent"])
    null_rates = [float(r) for r in spec["null_rates"]]
    nums, cats, codes, cat_cards = {}, {}, {}, {}
    for i in range(int(spec["n_numeric"])):
        v = np.floor(np.exp(rng.normal(1.0 + 0.25 * (i % 5),
                                       1.0 + 0.1 * (i % 3), size=n)))
        nums[f"i{i + 1}"] = v.astype(np.float64)
    ranks_of = []
    for j, card in enumerate(cards):
        ranks = np.searchsorted(_zipf_cdf(card, exponent),
                                rng.uniform(size=n)).astype(np.int64)
        ranks = np.minimum(ranks, card - 1)
        null = rng.uniform(size=n) < null_rates[j]
        name = f"c{j + 1}"
        cats[name] = _strings(j, ranks, null)
        codes[name] = np.where(null, -1, ranks)
        cat_cards[name] = card
        ranks_of.append((ranks, null))
    lab = spec["label"]
    logit = np.zeros(n)
    for i, coef in zip(lab["numeric_columns"], lab["numeric_coefs"]):
        x = np.log1p(nums[f"i{i + 1}"])
        logit += float(coef) * (x - x.mean())
    for j in lab["categorical_columns"]:
        ranks, null = ranks_of[j]
        logit += np.where(null, 0.0, category_effect(
            j, ranks, float(lab["categorical_scale"])))
    logit += float(lab["intercept"])
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-logit))
         ).astype(np.float64)
    return Table(nums=nums, cats=cats, cat_codes=codes, cat_cards=cat_cards,
                 label=y)
