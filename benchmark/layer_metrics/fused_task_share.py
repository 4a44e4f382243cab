"""Admission scheduler: of the tasks served in the window, the share
that a fused launch served, one program computing several statements'
answers from one scan (``/sched`` ``fused_tasks`` over ``tasks_done``),
in percent.  Nothing to read where the program counts neither."""


def read(run, arg=None):
    if "fused_tasks" not in run.sched_after:
        return None
    n = run.sched_delta("tasks_done")
    return 100.0 * run.sched_delta("fused_tasks") / n if n else None
