"""The median, over ten slices of the window, of each slice's 95th percentile
of (statement time / the median of the same statement text over the
window), pooled over the cell's statements: the tail a user feels, whatever
the class (``stats.stmt_p95_x`` says why in slices).  Fewer than 200
readings is an error and not a number."""

from harness import stats


def read(run, arg=None):
    return stats.stmt_p95_x(run.readings())
