"""Covertype-shaped data: 10 quantitative, 4 + 40 binary columns, 7 classes.

The schema of the UCI Covertype table (``covtype.info``): ten cartographic
measurements as whole numbers in their published ranges, one of four
wilderness areas and one of forty soil types a row as one-hot 0/1 columns,
and the cover type as the label (classes 0..6 for the source's 1..7).

Area and soil counts are QUOTAS of the row count (largest remainders of the
configured shares, no soil type under its floor) laid out by a seeded
permutation, not per-row draws: every table of a size has the very same
column supports whatever its seed or stream, so SanityChecker keeps the same
54 columns and no compiled shape follows the draw. The label is drawn a row
from a softmax: each class a band of elevation (centre and width from the
configuration, the bands shifted by the area), a preferred aspect (a cosine
of the bearing), linear effects of the three horizontal distances, and
fixed per-area and per-soil effects; the intercepts are solved on the table's own
rows so that the expected class shares are the published ones.
"""

from __future__ import annotations

import numpy as np

from chipbench.data import Table, seeded

#: the per-area and per-soil class effects are the same numbers in every
#: table: drawn once from this fixed stream, not from the run's seed
_EFFECT_STREAM = 581012


def quotas(shares, n: int, floor: int = 0) -> np.ndarray:
    """``n`` rows split by ``shares``: largest remainders, then every count
    raised to ``floor`` at the cost of the largest."""
    p = np.asarray(shares, np.float64)
    exact = p / p.sum() * n
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    need = np.maximum(floor - counts, 0)
    counts += need
    counts[int(np.argmax(counts))] -= int(need.sum())
    return counts


def _quantitative(rng, n: int, col: dict) -> np.ndarray:
    """One column as whole numbers in ``[lo, hi]`` with about the stated
    mean and spread: ``normal``, ``gamma`` (right-skewed from ``lo``),
    ``gamma_down`` (left-skewed from ``hi``) or ``uniform``."""
    lo, hi, mean, sd = (float(col[k]) for k in ("lo", "hi", "mean", "sd"))
    shape = col["shape"]
    if shape == "uniform":
        v = rng.uniform(lo, hi, size=n)
    elif shape == "normal":
        v = rng.normal(mean, sd, size=n)
    else:
        m = mean - lo if shape == "gamma" else hi - mean
        g = rng.gamma((m / sd) ** 2, sd * sd / m, size=n)
        v = lo + g if shape == "gamma" else hi - g
    return np.clip(np.rint(v), lo, hi)


def _one_hot_quota(rng, n: int, counts: np.ndarray) -> np.ndarray:
    """Category of each row: ``counts[j]`` rows of category ``j``, laid out
    by a permutation."""
    return np.repeat(np.arange(counts.size), counts)[rng.permutation(n)]


def class_logits(quant: dict, area, soil, spec: dict) -> np.ndarray:
    """``[n, K]`` logits without intercepts."""
    lab = spec["label"]
    K = len(spec["class_counts"])
    # a class is a band of elevation, which lies higher or lower by the area
    elev = quant[lab["band_column"]] - np.asarray(
        lab["band_shift_by_area"], np.float64)[area]
    z = -0.5 * ((elev[:, None] - np.asarray(lab["band_centers"])[None, :])
                / np.asarray(lab["band_widths"])[None, :]) ** 2
    for name, wave in lab["circular"].items():     # a compass bearing
        turn = np.deg2rad(quant[name][:, None]
                          - np.asarray(wave["phase"], np.float64)[None, :])
        z += np.asarray(wave["amplitude"], np.float64)[None, :] * np.cos(turn)
    by_name = {c["name"]: c for c in spec["quantitative"]}
    for name, coefs in lab["linear"].items():
        c = by_name[name]
        x = (quant[name] - float(c["mean"])) / float(c["sd"])
        z += x[:, None] * np.asarray(coefs, np.float64)[None, :]
    fx = np.random.default_rng(_EFFECT_STREAM)
    area_fx = float(lab["area_scale"]) * fx.normal(
        size=(len(spec["area_counts"]), K))
    soil_fx = float(lab["soil_scale"]) * fx.normal(
        size=(len(spec["soil_shares"]), K))
    return z + area_fx[area] + soil_fx[soil]


def _calibrate(z: np.ndarray, target: np.ndarray, rounds: int = 100):
    """The rows' class probabilities under the intercepts at which their
    mean is ``target`` (fixed-point iteration on the log shares, to
    1e-9)."""
    b = np.log(target)
    for _ in range(rounds):
        p = np.exp(z + b - (z + b).max(axis=1, keepdims=True))
        p /= p.sum(axis=1, keepdims=True)
        step = np.log(target / np.maximum(p.mean(axis=0), 1e-300))
        if np.abs(step).max() < 1e-9:
            break
        b += step
    return p


def make(n: int, seed: int, spec: dict, stream: int = 0) -> Table:
    rng = seeded(seed, stream)
    quant = {c["name"]: _quantitative(rng, n, c)
             for c in spec["quantitative"]}
    full = float(spec["published_rows"])
    floor = max(2, int(np.ceil(float(spec["soil_min_rows"]) * n / full)))
    area = _one_hot_quota(rng, n, quotas(spec["area_counts"], n))
    soil = _one_hot_quota(rng, n, quotas(spec["soil_shares"], n, floor))
    nums = dict(quant)
    for j in range(len(spec["area_counts"])):
        nums[f"Wilderness_Area{j + 1}"] = (area == j).astype(np.float64)
    for j in range(len(spec["soil_shares"])):
        nums[f"Soil_Type{j + 1}"] = (soil == j).astype(np.float64)
    target = np.asarray(spec["class_counts"], np.float64)
    p = _calibrate(class_logits(quant, area, soil, spec),
                   target / target.sum())
    u = rng.uniform(size=n)
    y = (u[:, None] > np.cumsum(p, axis=1)[:, :-1]).sum(axis=1)
    return Table(nums=nums, label=y.astype(np.float64))
