"""Largest ``peak_bytes_in_use`` over the cell's devices after the window,
as the device reports it: what a speed-up paid in memory."""


def read(run, arg=None):
    return run.memory_peak_bytes / 1e9 or None
