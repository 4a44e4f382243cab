"""Device programs: of the launches in the window whose program holds a
lookup join, the share whose join compacted its live probe rows to a
static capacity before the lookup, so that the gather cost the rows a
filter left and not the rows scanned (``/sched`` ``join_compact_launches``
over ``join_launches``).  Has to read 100 in ``tpch1x1.partjoin``: both of
its classes filter the fact side beneath the join.  Nothing to read where
no such program launched, or where the program keeps no such counter."""


def read(run, arg=None):
    if "join_compact_launches" not in run.sched_after:
        return None
    n = run.sched_delta("join_launches")
    return 100.0 * run.sched_delta("join_compact_launches") / n \
        if n else None
