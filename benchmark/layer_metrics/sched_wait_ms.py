"""Admission scheduler: median queue wait of its latest tasks, ``/sched``
``wait_p50_ms`` read at the window's end (host clock, the program's)."""


def read(run, arg=None):
    return run.sched_after.get("wait_p50_ms")
