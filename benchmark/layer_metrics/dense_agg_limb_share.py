"""Device programs: of the launches in the window whose program has a
DENSE aggregation at its root, the share whose integer SUM and COUNT states
were all reduced as int32 limb lanes in one pass over the rows (``/sched``
``dense_agg_limb_launches`` over ``dense_agg_launches``).  Has to read 100
on a TPU.  Nothing to read where no such program launched, or where the
program keeps no such counters."""


def read(run, arg=None):
    if "dense_agg_launches" not in run.sched_after:
        return None
    n = run.sched_delta("dense_agg_launches")
    return 100.0 * run.sched_delta("dense_agg_limb_launches") / n \
        if n else None
