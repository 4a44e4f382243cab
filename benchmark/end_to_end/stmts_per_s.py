"""Correct statements completed inside the window over the window's
length, all clients together."""


def read(run, arg=None):
    return run.completed_in_window() / (run.t_end - run.t0)
