"""Compile: programs compiled inside the window, the growth of ``/sched``
``compile_cache.misses``.  Has to read 0."""


def read(run, arg=None):
    return run.sched_delta("compile_cache", "misses")
