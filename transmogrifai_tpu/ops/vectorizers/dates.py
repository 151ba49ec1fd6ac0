"""Date/time vectorization onto the unit circle.

Parity: reference ``core/.../stages/impl/feature/DateToUnitCircleTransformer
.scala`` — a timestamp maps to (sin, cos) of its phase within a time period
(HourOfDay, DayOfWeek, DayOfMonth, DayOfYear, HourOfWeek, MonthOfYear,
WeekOfMonth, WeekOfYear), so midnight and 23:59 are neighbors.

TPU-first: the phase extraction is pure modular arithmetic, jittable and
fused — no calendar library on the hot path. Epoch milliseconds are 131 s
apart in float32 at 2016, so a date column reaches the device as whole days
since the epoch and milliseconds into the day as well (``frame.day_parts``),
and the phase is taken from those: the days modulo the period in int32 (every
period is a whole number of milliseconds, a ratio of small integers in
days), then the time of day in float32. Month-anchored
periods (DayOfMonth, MonthOfYear, WeekOfMonth) use the mean Gregorian month
(30.436875 days); the cyclic encoding is phase-accurate to within leap-drift,
which is what the model consumes. Missing dates encode as the circle center
(0,0) + a null indicator column.
"""

from __future__ import annotations

from fractions import Fraction
from typing import Optional

import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.stages.base import DeviceTransformer
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.vector_metadata import (
    parent_of,
    NULL_INDICATOR, VectorColumnMetadata, VectorMetadata,
)

__all__ = ["DateToUnitCircleVectorizer", "TIME_PERIODS"]

_MS_HOUR = 3600_000.0
_MS_DAY = 86_400_000.0
_MS_WEEK = 7 * _MS_DAY
_MS_MONTH = 30.436875 * _MS_DAY
_MS_YEAR = 365.2425 * _MS_DAY

# period -> (modulus ms, phase offset ms). Epoch 1970-01-01 was a Thursday;
# offset aligns DayOfWeek phase 0 to Monday.
TIME_PERIODS: dict[str, tuple[float, float]] = {
    "HourOfDay": (_MS_DAY, 0.0),
    "DayOfWeek": (_MS_WEEK, 3 * _MS_DAY),
    "HourOfWeek": (_MS_WEEK, 3 * _MS_DAY),
    "DayOfMonth": (_MS_MONTH, 0.0),
    "WeekOfMonth": (_MS_MONTH, 0.0),
    "MonthOfYear": (_MS_YEAR, 0.0),
    "DayOfYear": (_MS_YEAR, 0.0),
    "WeekOfYear": (_MS_YEAR, 0.0),
}


def _in_days(period: str) -> tuple[int, int, int]:
    """``(num, den, offset)`` of a period: its modulus is ``num / den``
    days and its offset ``offset / den`` days, all whole numbers."""
    modulus, offset = TIME_PERIODS[period]
    span = Fraction(round(modulus), fr.MS_PER_DAY)
    shift = Fraction(round(offset), fr.MS_PER_DAY) * span.denominator
    if shift.denominator != 1:
        raise ValueError(f"{period}: offset is no whole number of 1/"
                         f"{span.denominator} days")
    return span.numerator, span.denominator, int(shift)


def _phase_of_parts(period: str, days, ms_of_day):
    """The phase in ``[0, 2 pi)`` of ``frame.day_parts``: the days modulo
    the period's ``num / den`` days exactly, in int32 (to day 1,342,177 at
    ``den`` 1,600), then the time of day added in float32 (one period
    spans at most 146,097 such units: 1e-7 of a turn)."""
    num, den, shift = _in_days(period)
    whole = jnp.mod(days.astype(jnp.int32) * den + shift, num)
    turn = whole.astype(jnp.float32) + ms_of_day * (den / fr.MS_PER_DAY)
    turn = jnp.where(turn >= num, turn - num, turn)
    return turn / num * (2.0 * np.pi)


class DateToUnitCircleVectorizer(DeviceTransformer):
    """N date inputs -> [sin, cos][, null] per input."""

    variadic = True
    in_types = (ft.Date,)
    out_type = ft.OPVector

    def __init__(self, time_period: str = "HourOfDay",
                 track_nulls: bool = True, uid: Optional[str] = None):
        if time_period not in TIME_PERIODS:
            raise ValueError(
                f"Unknown time period {time_period!r}; one of {sorted(TIME_PERIODS)}")
        self.time_period = time_period
        self.track_nulls = track_nulls
        super().__init__(uid=uid)

    def _phase(self, ms):
        modulus, offset = TIME_PERIODS[self.time_period]
        return ((ms + offset) % modulus) / modulus * (2.0 * np.pi)

    def device_apply(self, params, *cols: fr.NumericColumn) -> fr.VectorColumn:
        pieces = []
        for c in cols:
            theta = (self._phase(c.values) if c.day_parts is None
                     else _phase_of_parts(self.time_period, *c.day_parts))
            pieces.append((jnp.sin(theta) * c.mask)[:, None])
            pieces.append((jnp.cos(theta) * c.mask)[:, None])
            if self.track_nulls:
                pieces.append((1.0 - c.mask)[:, None])
        meta = self._meta()
        return fr.VectorColumn(jnp.concatenate(pieces, axis=1), meta)

    def transform_row(self, *values):
        out = []
        for v in values:
            if v is None:
                out.extend([0.0, 0.0])
            else:
                theta = float(self._phase(np.float64(v)))
                out.extend([np.sin(theta), np.cos(theta)])
            if self.track_nulls:
                out.append(1.0 if v is None else 0.0)
        return np.asarray(out, dtype=np.float32)

    def _meta(self) -> VectorMetadata:
        cols = []
        for f in self.input_features:
            for part in ("sin", "cos"):
                cols.append(VectorColumnMetadata(
                    *parent_of(f), grouping=f.name,
                    descriptor_value=f"{part}_{self.time_period}"))
            if self.track_nulls:
                cols.append(VectorColumnMetadata(
                    *parent_of(f), grouping=f.name,
                    indicator_value=NULL_INDICATOR))
        return VectorMetadata(self.get_output().name, tuple(cols)).reindexed(0)
