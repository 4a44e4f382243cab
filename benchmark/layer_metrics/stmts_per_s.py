"""Admission scheduler: correct statements completed inside the window
over the window's length, all streams together (host clock, the load
generator's): what a server with several statements in flight sustains.
The per-layer twin of ``end_to_end/stmts_per_s.py``, for the cells with
more than one client."""


def read(run, arg=None):
    length = run.t_end - run.t0
    return run.completed_in_window() / length if length > 0 else None
