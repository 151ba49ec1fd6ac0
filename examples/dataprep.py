"""Data-prep examples: conditional aggregation + joins-and-aggregates.

Parity: reference ``helloworld/.../dataprep/{ConditionalAggregation,
JoinsAndAggregates}.scala`` over the REAL datasets the reference ships
(``WebVisitsDataset/WebVisits.csv``, ``EmailDataset/{Clicks,Sends}.csv``),
reproducing the expected outputs printed in those files:

- conditional: per-user cutoff at the first SaveBig landing-page visit;
  visits the week BEFORE are predictors, purchases the day AFTER the
  response (ConditionalAggregation.scala expected table).
- joins: clicks/sends aggregate readers (cutoff 2017-09-04) left-outer
  joined by user; CTR derived across the two tables via the feature DSL
  (JoinsAndAggregates.scala expected table).

Run: python examples/dataprep.py
"""

from __future__ import annotations

import os
import sys
from datetime import datetime, timezone

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from transmogrifai_tpu import dsl  # noqa: F401 — installs the feature DSL
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.readers import DataReaders
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.utils.compile_cache import enable_compile_cache

_RES = "/root/reference/helloworld/src/main/resources"
WEB_VISITS_CSV = f"{_RES}/WebVisitsDataset/WebVisits.csv"
CLICKS_CSV = f"{_RES}/EmailDataset/Clicks.csv"
SENDS_CSV = f"{_RES}/EmailDataset/Sends.csv"

DAY_MS = 86_400_000


def ts_ms(s: str) -> int:
    """'2017-09-01::10:00:00' -> epoch ms (reference joda pattern)."""
    return int(datetime.strptime(s, "%Y-%m-%d::%H:%M:%S")
               .replace(tzinfo=timezone.utc).timestamp() * 1000)


#: CutOffTime.DDMMYYYY("04092017")
CUTOFF_MS = ts_ms("2017-09-04::00:00:00")


# -- module-level extract fns (serializable contract) ------------------------

def one(_row) -> float:
    return 1.0


def purchase_indicator(row) -> float:
    return 1.0 if row.get("productId") not in (None, "") else 0.0


def is_savebig(row) -> bool:
    return row.get("url") == "http://www.amazon.com/SaveBig"


def visit_time(row) -> int:
    return ts_ms(row["timestamp"])


def conditional_aggregation():
    """ConditionalAggregation.scala: predict purchases within a day of the
    SaveBig landing visit from the visit count the week before."""
    # RealNN's default monoid is SUM (reference SumRealNN)
    num_visits_week_prior = (FeatureBuilder.RealNN("numVisitsWeekPrior")
                             .extract(one)
                             .window(7 * DAY_MS).as_predictor())
    num_purchases_next_day = (FeatureBuilder.RealNN("numPurchasesNextDay")
                              .extract(purchase_indicator)
                              .window(1 * DAY_MS).as_response())
    reader = DataReaders.Conditional.csv(
        WEB_VISITS_CSV,
        schema={"userId": ft.Text, "url": ft.Text, "productId": ft.Text,
                "price": ft.Real, "timestamp": ft.Text},
        header=False,
        columns=["userId", "url", "productId", "price", "timestamp"],
        key_fn=lambda r: r["userId"],
        time_fn=visit_time,
        condition_fn=is_savebig)
    return reader.generate_frame([num_visits_week_prior,
                                  num_purchases_next_day])


def click_time(row) -> int:
    return ts_ms(row["timeStamp"])


def joins_and_aggregates():
    """JoinsAndAggregates.scala: clicks/sends aggregate readers joined by
    user; CTR derived across the two tables."""
    num_clicks_yday = (FeatureBuilder.Real("numClicksYday")
                       .extract(one).source("clicks")
                       .window(1 * DAY_MS).as_predictor())
    num_sends_last_week = (FeatureBuilder.Real("numSendsLastWeek")
                           .extract(one).source("sends")
                           .window(7 * DAY_MS).as_predictor())
    num_clicks_tomorrow = (FeatureBuilder.Real("numClicksTomorrow")
                           .extract(one).source("clicks")
                           .window(1 * DAY_MS).as_response())
    ctr = (num_clicks_yday / (num_sends_last_week + 1.0)).alias("ctr")

    click_schema = {"clickId": ft.Integral, "userId": ft.Text,
                    "emailId": ft.Integral, "timeStamp": ft.Text}
    send_schema = {"sendId": ft.Integral, "userId": ft.Text,
                   "emailId": ft.Integral, "timeStamp": ft.Text}
    clicks = DataReaders.Aggregate.csv(
        CLICKS_CSV, schema=click_schema, header=False,
        columns=list(click_schema), key_fn=lambda r: r["userId"],
        time_fn=click_time, cutoff_ms=CUTOFF_MS).with_source_tag("clicks")
    sends = DataReaders.Aggregate.csv(
        SENDS_CSV, schema=send_schema, header=False,
        columns=list(send_schema), key_fn=lambda r: r["userId"],
        time_fn=click_time, cutoff_ms=CUTOFF_MS).with_source_tag("sends")
    joined = sends.left_outer_join(clicks)
    # ctr is DERIVED (divide over the two tables): route through the
    # workflow like the reference (raw lineage pulls the joined reader)
    from transmogrifai_tpu.workflow import Workflow
    model = (Workflow().set_reader(joined)
             .set_result_features(num_clicks_yday, num_clicks_tomorrow,
                                  num_sends_last_week, ctr).train())
    return model.score(joined)


def main() -> int:
    enable_compile_cache()
    cond = conditional_aggregation()
    print("ConditionalAggregation:")
    for i in range(cond.n_rows):
        print(" ", cond.key[i], cond.row(i))
    joined = joins_and_aggregates()
    print("JoinsAndAggregates:")
    for i in range(joined.n_rows):
        print(" ", joined.key[i], joined.row(i))
    return 0


if __name__ == "__main__":
    sys.exit(main())
