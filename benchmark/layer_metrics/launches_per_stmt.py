"""Admission scheduler: device launches in the window over statements
answered.  1.0 where nothing coalesces or fuses; below it where it does."""


def read(run, arg=None):
    n = len(run.answered())
    return run.sched_delta("launches") / n if n else None
