"""Geometric mean, over the cell's classes, of each class's typical
client-side statement time in the window: the mean over its parameter sets
of each set's median (host clock of the load generator's process, send to
last row decoded)."""

from harness import stats


def read(run, arg=None):
    return stats.stmt_ms_geomean(run.ms())
