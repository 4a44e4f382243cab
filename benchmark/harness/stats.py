"""Metric arithmetic on the load generator's records.  Pure Python, so the
selftest can feed it made-up readings."""

from __future__ import annotations

import math
from statistics import median, quantiles

MIN_TAIL_READINGS = 200
TAIL_SLICES = 10


class TooFewReadings(ValueError):
    """A tail was asked of fewer readings than it needs."""


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` of
    the readings at or below it."""
    if not values:
        raise TooFewReadings("no readings")
    ordered = sorted(values)
    return ordered[max(math.ceil(q * len(ordered)) - 1, 0)]


def geomean(values: list[float]) -> float:
    if not values or min(values) <= 0:
        raise ValueError(f"geometric mean of {values}")
    return math.exp(sum(math.log(v) for v in values) / len(values))


def class_ms(by_statement: dict[int, list[float]]) -> float:
    """A class's typical statement time: the mean, over its parameter
    sets, of each set's median.  One median over the whole class would
    jump between the modes where parameter sets differ in cost (Q6's
    device time is 4.6 or 6.3 ms by its discount literal)."""
    medians = [median(v) for v in by_statement.values() if v]
    return sum(medians) / len(medians)


def stmt_ms_geomean(ms: dict[str, dict[int, list[float]]]) -> float:
    """Geometric mean over classes of each class's typical statement time
    (``class_ms``): a tenth off the shortest class counts as much as a
    tenth off the longest, the shape of TPC-H's power metric.  ``ms`` is
    ``{class: {statement: [ms, ...]}}``."""
    return geomean([class_ms(v) for v in ms.values() if v])


def stmt_p95_x(readings: list[tuple[float, int, float]]) -> float:
    """The tail a user feels, whatever the class.  ``readings`` is one
    ``(due, statement, ms)`` per statement answered.  Each time is divided
    by the median of the same statement text over the whole window; the
    readings, in the order they were due, are cut into ``TAIL_SLICES``
    runs of equal count; each run gives its 95th percentile, and the
    result is the median of those.

    One percentile over the whole window moved with whatever the host did
    to a twentieth of the statements: on a one-chip machine, whose CPU
    cores are shared, its spread over six runs of the same code was 0.7 %
    in one set and 4.7 % in the next.  The median over slices gives way
    only when half the window is disturbed, as a median does.  It is blind
    to a tail that sits in fewer than half of the slices, as the single
    percentile is to one in fewer than a twentieth of the statements.

    Fewer than 200 readings is an error: a slice's tail would rest on its
    largest reading alone."""
    if len(readings) < MIN_TAIL_READINGS:
        raise TooFewReadings(
            f"stmt_p95_x needs {MIN_TAIL_READINGS} readings, the window "
            f"gave {len(readings)}")
    by_statement: dict[int, list[float]] = {}
    for _, stmt, ms in readings:
        by_statement.setdefault(stmt, []).append(ms)
    typical = {stmt: median(v) for stmt, v in by_statement.items()}
    x = [ms / typical[stmt]
         for _, stmt, ms in sorted(readings, key=lambda r: r[0])]
    cuts = [len(x) * k // TAIL_SLICES for k in range(TAIL_SLICES + 1)]
    return median(percentile(x[lo:hi], 0.95)
                  for lo, hi in zip(cuts, cuts[1:]))


def spread(values: list[float]) -> float:
    """Distance between the quartiles over the median: what a bound is set
    from (five times the widest spread, never under 1 %)."""
    q1, _, q3 = quantiles(values, n=4, method="inclusive")
    return (q3 - q1) / median(values)
