"""Device programs: of the statements answered in the window, the share
that one launch of a join-carrying device program answered (``/sched``
``join_launches``), none of them by the repartition join
(``join_shuffle_launches``) and none by a lookup join's host fallback
(``join_host_fallbacks``).  Has to read 100 in a cell whose every
statement is a broadcast lookup join.  Nothing to read on a program that
keeps no such counters."""


def read(run, arg=None):
    if "join_launches" not in run.sched_after:
        return None
    answered = len(run.answered())
    if not answered:
        return None
    if run.sched_delta("join_shuffle_launches") \
            or run.sched_delta("join_host_fallbacks"):
        return 0.0
    return 100.0 * run.sched_delta("join_launches") / answered
