"""Wire: what the client waits beyond the server's own clock.  Per class,
the mean client-side statement time minus the mean the server recorded for
the same statements in ``information_schema.statements_summary`` (which
keeps sums, so means and not medians); geometric mean over classes."""

from harness import stats


def read(run, arg=None):
    ms, per_class = run.ms_by_class(), []
    for cls, (n1, sum1) in run.summary_after.items():
        n0, sum0 = run.summary_before.get(cls, (0, 0.0))
        if n1 > n0 and ms.get(cls):
            server = (sum1 - sum0) / (n1 - n0)
            per_class.append(sum(ms[cls]) / len(ms[cls]) - server)
    if not per_class or min(per_class) <= 0:
        return None
    return stats.geomean(per_class)
