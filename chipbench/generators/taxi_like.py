"""NYC-taxi-shaped trips: the schema of the Kaggle "New York City Taxi Trip
Duration" ``train.csv`` (2016 yellow-cab trips from the NYC TLC), typed.

A row: ``vendor_id`` ("1" or "2"), ``pickup_datetime`` (epoch milliseconds,
whole seconds, 2016-01-01 to 2016-06-30), ``passenger_count``, the pickup
and dropoff coordinates, ``store_and_fwd_flag`` ("N" or "Y"), and the label
``log1p(trip_duration)`` (seconds). ``id`` and ``dropoff_datetime`` are not
made: the configuration leaves them out (the second is the label).

The columns SanityChecker decides on are FIXED QUOTAS of the row count laid
out by a seeded permutation (vendor, flag, passenger count), so every table
of a size has the same column supports whatever its seed or stream. Pickup
times follow an hour-of-day and a weekday volume profile; places are drawn
from a Manhattan band along the island's axis, the two airports and two
outer boroughs; a dropoff follows its pickup (a local trip of log-normal
length, an airport run, a trip between boroughs). The duration is the
haversine distance over an hour- and weekday-dependent speed that grows with
the trip's length, a pickup overhead, a curbside term at the airports and
log-normal noise with a few detours.
"""

from __future__ import annotations

import numpy as np

from chipbench.data import Table, seeded
from chipbench.generators.covtype_like import quotas

_KM_PER_DEG_LAT = 111.2
_EARTH_KM = 6371.0


def _one_of(rng, n: int, counts) -> np.ndarray:
    """Category of each row: ``counts[j]`` rows of category ``j``, laid out
    by a permutation."""
    return np.repeat(np.arange(len(counts)), counts)[rng.permutation(n)]


def _pickup_ms(rng, n: int, spec: dict) -> np.ndarray:
    """Epoch milliseconds of whole seconds: a day of the range weighted by
    its weekday's volume, an hour by the hour-of-day profile, a second
    uniform in the hour."""
    t = spec["time"]
    day0 = int(t["first_day_epoch"])
    days = np.arange(int(t["days"]))
    weekday = (day0 + days + 3) % 7                     # 0 = Monday
    p_day = np.asarray(t["weekday_volume"], np.float64)[weekday]
    day = day0 + rng.choice(days, size=n, p=p_day / p_day.sum())
    hours = np.asarray(t["hour_volume"], np.float64)
    hour = rng.choice(24, size=n, p=hours / hours.sum())
    second = rng.integers(0, 3600, size=n)
    return (day * 86_400 + hour * 3600 + second).astype(np.float64) * 1000.0


def _offset(center, north_km, east_km):
    lat0, lon0 = center
    return (lat0 + north_km / _KM_PER_DEG_LAT,
            lon0 + east_km / (_KM_PER_DEG_LAT * np.cos(np.deg2rad(lat0))))


def _places(rng, n: int, name: str, geo: dict):
    """``(lat, lon)`` of ``n`` points of place ``name``: the Manhattan band
    (along its axis a mixture of Midtown and Downtown, across it narrow), or
    a cluster about a centre."""
    if name == "manhattan":
        band = geo["manhattan"]
        mid = rng.uniform(size=n) < band["midtown_share"]
        u = np.where(mid, rng.normal(0.0, band["midtown_sd_km"], n),
                     rng.normal(band["downtown_km"], band["downtown_sd_km"],
                                n))
        u = np.clip(u, band["axis_km"][0], band["axis_km"][1])
        v = np.clip(rng.normal(0.0, band["across_sd_km"], n),
                    -band["across_max_km"], band["across_max_km"])
        a = np.deg2rad(band["axis_deg"])
        return _offset(band["center"], u * np.cos(a) - v * np.sin(a),
                       u * np.sin(a) + v * np.cos(a))
    c = geo["clusters"][name]
    return _offset(c["center"], rng.normal(0.0, c["sd_km"][0], n),
                   rng.normal(0.0, c["sd_km"][1], n))


def _local(rng, lat, lon, median_km: float, sigma: float, axis_deg: float,
           along_share: float):
    """A dropoff a log-normal distance from each pickup, mostly along the
    island's axis (either way), else in any direction."""
    n = lat.size
    dist = median_km * np.exp(rng.normal(0.0, sigma, n))
    along = rng.uniform(size=n) < along_share
    bearing = np.where(
        along, np.deg2rad(axis_deg) + np.pi * (rng.uniform(size=n) < 0.5)
        + rng.normal(0.0, np.deg2rad(15.0), n),
        rng.uniform(0.0, 2 * np.pi, n))
    return _offset((lat, lon), dist * np.cos(bearing), dist * np.sin(bearing))


def haversine_km(lat1, lon1, lat2, lon2) -> np.ndarray:
    p1, p2 = np.deg2rad(lat1), np.deg2rad(lat2)
    dp, dl = p2 - p1, np.deg2rad(lon2 - lon1)
    h = np.sin(dp / 2) ** 2 + np.cos(p1) * np.cos(p2) * np.sin(dl / 2) ** 2
    return 2.0 * _EARTH_KM * np.arcsin(np.sqrt(np.minimum(h, 1.0)))


def _trips(rng, n: int, geo: dict):
    """Pickup and dropoff coordinates, and whether an airport is either
    end."""
    names = list(geo["pickup_share"])
    start = rng.choice(len(names), size=n,
                       p=np.asarray([geo["pickup_share"][k] for k in names]))
    lat0, lon0 = np.empty(n), np.empty(n)
    lat1, lon1 = np.empty(n), np.empty(n)
    airport = np.zeros(n, bool)
    band = geo["manhattan"]
    for i, name in enumerate(names):
        rows = np.nonzero(start == i)[0]
        lat0[rows], lon0[rows] = _places(rng, rows.size, name, geo)
        to = geo["dropoff_given_pickup"][name]
        ends = list(to)
        end = rng.choice(len(ends), size=rows.size,
                         p=np.asarray([to[k] for k in ends]))
        for j, dest in enumerate(ends):
            r = rows[end == j]
            if dest == "local":
                trip = geo["local_trip"]
                lat1[r], lon1[r] = _local(
                    rng, lat0[r], lon0[r], trip["median_km"], trip["sigma"],
                    band["axis_deg"], trip["along_axis_share"])
            else:
                lat1[r], lon1[r] = _places(rng, r.size, dest, geo)
            airport[r] = (name in geo["airports"]) | (dest in geo["airports"])
    return lat0, lon0, lat1, lon1, airport


def _duration_s(rng, ms, km, airport, spec: dict) -> np.ndarray:
    lab = spec["label"]
    sec = (ms // 1000).astype(np.int64)
    hour = (sec // 3600) % 24
    weekday = (sec // 86_400 + 3) % 7
    weekend = weekday >= 5
    speed = np.where(weekend, np.asarray(lab["weekend_speed_kmh"])[hour],
                     np.asarray(lab["weekday_speed_kmh"])[hour])
    speed = speed * (1.0 + lab["highway_gain"]
                     * np.log1p(km / lab["highway_km"]))
    t = lab["overhead_s"] + km / speed * 3600.0 \
        + lab["airport_s"] * airport
    t = t * np.exp(rng.normal(0.0, lab["noise_sigma"], km.size))
    detour = rng.uniform(size=km.size) < lab["detour_share"]
    t = t * np.where(detour, np.exp(rng.uniform(0.0, lab["detour_max_log"],
                                                km.size)), 1.0)
    return np.clip(np.rint(t), 1.0, lab["max_s"])


def make(n: int, seed: int, spec: dict, stream: int = 0) -> Table:
    rng = seeded(seed, stream)
    q = spec["quotas"]
    vendor = _one_of(rng, n, quotas(q["vendor_id"]["shares"], n))
    flag = _one_of(rng, n, quotas(q["store_and_fwd_flag"]["shares"], n))
    full = float(spec["published_rows"])
    floor = max(1, int(np.ceil(q["passenger_count"]["min_rows"] * n / full)))
    passengers = _one_of(rng, n, quotas(q["passenger_count"]["shares"], n,
                                        floor))
    ms = _pickup_ms(rng, n, spec)
    lat0, lon0, lat1, lon1, airport = _trips(rng, n, spec["geo"])
    km = haversine_km(lat0, lon0, lat1, lon1)
    y = np.log1p(_duration_s(rng, ms, km, airport, spec))
    vendors = np.asarray(q["vendor_id"]["values"], dtype=object)
    flags = np.asarray(q["store_and_fwd_flag"]["values"], dtype=object)
    nums = {"pickup_datetime": ms,
            "passenger_count": np.asarray(q["passenger_count"]["values"],
                                          np.float64)[passengers],
            "pickup_longitude": lon0, "pickup_latitude": lat0,
            "dropoff_longitude": lon1, "dropoff_latitude": lat1}
    return Table(nums=nums,
                 cats={"vendor_id": vendors[vendor],
                       "store_and_fwd_flag": flags[flag]},
                 cat_codes={"vendor_id": vendor, "store_and_fwd_flag": flag},
                 cat_cards={"vendor_id": len(vendors),
                            "store_and_fwd_flag": len(flags)},
                 label=y)
