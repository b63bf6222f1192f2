"""Airline on-time table in the schema of NVIDIA gbm-bench's ``airline``
set: 13 float32 features a flight and a 0/1 label, ``ArrDelay > 0``.

The columns, in gbm-bench's order (the categorical ones as integer
codes): Year, Month, DayofMonth, DayofWeek, CRSDepTime, CRSArrTime,
UniqueCarrier, FlightNum, ActualElapsedTime, Origin, Dest, Distance,
Diverted.  Each is drawn with the range and cardinality the
configuration's ``generator_params`` give (times as hhmm, carriers and
airports Zipf-popular, Distance log-normal, the elapsed time following
the distance, Diverted rare).  The label is a Bernoulli draw of a
logistic of a few interacting columns (the departure hour, the month,
the carrier, the origin airport late in the day, the day of the week,
the year, the distance, a diversion), so that trees find real splits;
its intercept is set so that about ``positive_share`` of the flights
are late.

Rows are drawn in ``PARTS`` fixed parts, each from its own child of
``SeedSequence(seed)``, on a few threads: the table does not depend on
how many threads ran.  Columns as ``GBTClassifier`` reads them:
``features`` ``(rows, 13)`` float32 C-contiguous, ``label`` float32.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = min(12, os.cpu_count() or 1)
PARTS = 48
COLUMNS = ("Year", "Month", "DayofMonth", "DayofWeek", "CRSDepTime",
           "CRSArrTime", "UniqueCarrier", "FlightNum", "ActualElapsedTime",
           "Origin", "Dest", "Distance", "Diverted")


def _zipf(count: int, exponent: float) -> np.ndarray:
    w = 1.0 / np.arange(1, count + 1) ** exponent
    return np.cumsum(w / w.sum())


def _draw(rng, cdf: np.ndarray, size: int) -> np.ndarray:
    return np.minimum(np.searchsorted(cdf, rng.random(size)), len(cdf) - 1)


def _hhmm(minutes: np.ndarray) -> np.ndarray:
    minutes = np.mod(minutes, 1440)
    return (minutes // 60) * 100 + minutes % 60


def generate(params: dict, seed: int) -> dict:
    rows = int(params["rows"])
    years = np.arange(int(params["first_year"]), int(params["last_year"]) + 1)
    carriers, airports = int(params["carriers"]), int(params["airports"])
    seeds = np.random.SeedSequence(int(seed)).spawn(PARTS + 1)
    model = np.random.default_rng(seeds[-1])
    # effects of the label's model, one draw a seed
    carrier_effect = model.normal(0.0, float(params["carrier_effect"]),
                                  carriers)
    hub_effect = model.normal(0.0, float(params["hub_effect"]), airports)
    year_cdf = np.cumsum(np.linspace(1.0, float(params["year_growth"]),
                                     len(years)))
    year_cdf /= year_cdf[-1]
    carrier_cdf = _zipf(carriers, float(params["carrier_zipf"]))
    airport_cdf = _zipf(airports, float(params["airport_zipf"]))
    features = np.empty((rows, len(COLUMNS)), np.float32)
    label = np.empty(rows, np.float32)
    bounds = np.linspace(0, rows, PARTS + 1).astype(np.int64)

    def part(i: int) -> None:
        rng = np.random.default_rng(seeds[i])
        lo, hi = bounds[i], bounds[i + 1]
        m = hi - lo
        year = years[_draw(rng, year_cdf, m)]
        month = rng.integers(1, 13, m)
        day = rng.integers(1, 32, m)
        weekday = rng.integers(1, 8, m)
        # departures: a day-long spread with morning and evening peaks
        peak = np.where(rng.random(m) < 0.5, 8 * 60, 17 * 60)
        dep = np.where(rng.random(m) < float(params["peak_share"]),
                       rng.normal(peak, 90.0),
                       rng.uniform(5 * 60, 22 * 60, m))
        dep = np.clip(np.rint(dep / 5.0) * 5.0, 0, 1435).astype(np.int64)
        distance = np.clip(np.rint(rng.lognormal(
            np.log(float(params["distance_median"])),
            float(params["distance_sigma"]), m)),
            float(params["distance_min"]), float(params["distance_max"]))
        elapsed = np.clip(np.rint(20.0 + distance / 7.5
                                  + rng.normal(0.0, 12.0, m)), 15, 700)
        # the arrival's clock time: in the destination's time zone
        arr = dep + elapsed.astype(np.int64) + 60 * rng.integers(-3, 4, m)
        carrier = _draw(rng, carrier_cdf, m)
        flight = rng.integers(1, int(params["flight_numbers"]), m)
        origin = _draw(rng, airport_cdf, m)
        dest = _draw(rng, airport_cdf, m)
        dest = np.where(dest == origin, (dest + 1) % airports, dest)
        diverted = rng.random(m) < float(params["diverted_share"])

        hour = dep / 60.0
        logit = (float(params["intercept"])
                 + 0.18 * (hour - 13.0)
                 + 0.45 * np.isin(month, (6, 7, 12))
                 + carrier_effect[carrier]
                 + hub_effect[origin] * (hour > 15.0)
                 + 0.3 * np.isin(weekday, (4, 5))
                 + 0.04 * np.maximum(year - 2002, 0)
                 - 0.15 * np.log(distance / float(params["distance_median"]))
                 + 3.0 * diverted)
        late = rng.random(m) < 1.0 / (1.0 + np.exp(-logit))
        out = features[lo:hi]
        for j, column in enumerate((year, month, day, weekday, _hhmm(dep),
                                    _hhmm(arr), carrier, flight, elapsed,
                                    origin, dest, distance, diverted)):
            out[:, j] = column
        label[lo:hi] = late

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(part, range(PARTS)))
    return {"features": features, "label": label}
