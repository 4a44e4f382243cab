"""Compile: seconds inside ``lower().compile()`` (or the persistent cache's
load) from process start to the window's start, ``/sched``
``compile_cache.compile_ms``."""


def read(run, arg=None):
    cc = run.sched_before.get("compile_cache")
    return cc["compile_ms"] / 1e3 if cc else None
