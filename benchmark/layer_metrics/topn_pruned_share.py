"""Device programs: of the launches in the window whose program has a
TopN root, the share whose TopN prunes by per-block minima instead of
sorting every row (``/sched`` ``topn_pruned_launches`` over
``topn_launches``).  Has to read 100 in a cell with resident tables.
Nothing to read where no such program launched, or where the program
keeps no such counters."""


def read(run, arg=None):
    if "topn_launches" not in run.sched_after:
        return None
    n = run.sched_delta("topn_launches")
    return 100.0 * run.sched_delta("topn_pruned_launches") / n if n else None
