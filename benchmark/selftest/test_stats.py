"""The end-to-end arithmetic on made-up readings."""

import math

import pytest

from harness import stats


def test_geomean_over_classes_of_mean_over_statements_of_medians():
    ms = {"a": {0: [10.0, 12.0, 11.0], 1: [13.0, 13.0, 99.0]},
          "b": {2: [100.0, 90.0, 110.0, 400.0]}}
    # a: medians 11 and 13, mean 12; b: 105; one long reading moves nothing
    assert stats.stmt_ms_geomean(ms) == pytest.approx(math.sqrt(12 * 105))


def test_class_time_does_not_jump_between_modes():
    # two parameter sets of different cost, as Q6's are: a few readings
    # more of one or the other do not move the class's time
    more_fast = {"q6": {0: [9.0] * 60, 1: [11.0] * 40}}
    more_slow = {"q6": {0: [9.0] * 40, 1: [11.0] * 60}}
    assert stats.stmt_ms_geomean(more_fast) == pytest.approx(10.0)
    assert stats.stmt_ms_geomean(more_slow) == pytest.approx(10.0)


def test_a_tenth_off_any_class_counts_the_same():
    base = {"a": {0: [10.0] * 3}, "b": {1: [400.0] * 3}}
    fast_a = {"a": {0: [9.0] * 3}, "b": {1: [400.0] * 3}}
    fast_b = {"a": {0: [10.0] * 3}, "b": {1: [360.0] * 3}}
    assert stats.stmt_ms_geomean(fast_a) == pytest.approx(
        stats.stmt_ms_geomean(fast_b))
    assert stats.stmt_ms_geomean(fast_a) < stats.stmt_ms_geomean(base)


def readings(ms_by_statement, every=1.0):
    """Made-up ``(due, statement, ms)``: the statements take turns, one
    reading each ``every`` seconds, each statement's times in the order
    given."""
    out, due = [], 0.0
    for k in range(max(len(v) for v in ms_by_statement.values())):
        for stmt, v in ms_by_statement.items():
            if k < len(v):
                out.append((due, stmt, v[k]))
                due += every
    return out


def spaced(typical, slow, n, k):
    """``n`` times, every ``k``-th one ``slow`` and the others ``typical``."""
    return [slow if i % k == k - 1 else typical for i in range(n)]


def test_p95_x_is_relative_to_the_same_statement_and_pooled():
    # one reading in 40 at twice its statement's median, all through the
    # window: under a twentieth, so no slice's 95th percentile sees it
    r = readings({0: spaced(10.0, 20.0, 200, 40),
                  1: spaced(300.0, 600.0, 200, 40)})
    assert stats.stmt_p95_x(r) == pytest.approx(1.0)
    # one in 5 of the short statement: a tenth of every slice's readings
    r = readings({0: spaced(10.0, 20.0, 200, 5),
                  1: spaced(300.0, 600.0, 200, 40)})
    assert stats.stmt_p95_x(r) == pytest.approx(2.0)
    # a dearer parameter set of the same class is not a tail
    assert stats.stmt_p95_x(readings({0: [9.0] * 150, 1: [11.0] * 150})) \
        == pytest.approx(1.0)
    # the order the readings come in does not matter, the time they were
    # due does
    assert stats.stmt_p95_x(r[::-1]) == pytest.approx(2.0)


def test_p95_x_is_the_median_over_slices_of_the_window():
    # 400 readings; a disturbance doubles every reading of three slices
    # of the ten (the host's doing: a neighbour on a shared core): one
    # percentile over the window would read 2.0, the typical slice's is 1.0
    r = readings({0: [10.0] * 140 + [20.0] * 60,
                  1: [50.0] * 140 + [100.0] * 60})
    assert stats.stmt_p95_x(r) == pytest.approx(1.0)
    assert stats.percentile([ms / (10.0 if s == 0 else 50.0)
                             for _, s, ms in r], 0.95) == 2.0
    # a tail in more than half of the slices is the cell's own
    r = readings({0: spaced(10.0, 30.0, 120, 10) + [10.0] * 80,
                  1: spaced(50.0, 150.0, 120, 10) + [50.0] * 80})
    assert stats.stmt_p95_x(r) == pytest.approx(3.0)


def test_p95_x_refuses_fewer_than_200_readings():
    with pytest.raises(stats.TooFewReadings):
        stats.stmt_p95_x(readings({0: [1.0] * 150, 1: [2.0] * 49}))
    assert stats.stmt_p95_x(readings({0: [1.0] * 150, 1: [2.0] * 50})) == 1.0


def test_percentile_is_nearest_rank():
    assert stats.percentile([1, 2, 3, 4], 0.5) == 2
    assert stats.percentile(list(range(1, 101)), 0.95) == 95
    with pytest.raises(stats.TooFewReadings):
        stats.percentile([], 0.95)


def test_spread_is_interquartile_over_median():
    assert stats.spread([10, 10, 10, 10, 10, 10]) == 0
    # quartiles 9.25 and 10.75 over a median of 10
    assert stats.spread([8, 9, 10, 10, 11, 12]) == pytest.approx(0.15)
