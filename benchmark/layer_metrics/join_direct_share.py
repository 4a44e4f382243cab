"""Device programs: of the launches in the window whose program holds a
lookup join, the share whose every lookup was direct-addressed (one
gather a probe row into a table over the build key's range) and none a
binary search of sorted keys or an expanding join (``/sched``
``join_direct_launches`` over ``join_launches``).  Has to read 100 in
``tpch1x1.orderjoin``: ``o_orderkey`` and ``c_custkey`` are unique keys
whose ranges a table spans, however sparse.  Nothing to read where no
such program launched, or where the program keeps no such counter."""


def read(run, arg=None):
    if "join_direct_launches" not in run.sched_after:
        return None
    n = run.sched_delta("join_launches")
    return 100.0 * run.sched_delta("join_direct_launches") / n \
        if n else None
