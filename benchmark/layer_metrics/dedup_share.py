"""Admission scheduler: of the tasks served in the window, the share
that shared another task's execution: same program, same resident
inputs, in flight together (``/sched`` ``dedup_tasks`` over
``tasks_done``), in percent.  It needs no group program and costs no
compile.  Nothing to read where the program keeps no such counter."""


def read(run, arg=None):
    if "dedup_tasks" not in run.sched_after:
        return None
    n = run.sched_delta("tasks_done")
    return 100.0 * run.sched_delta("dedup_tasks") / n if n else None
