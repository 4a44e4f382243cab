"""Process start to the first statement of the window: import, server,
generate, register, ANALYZE, H2D, oracle, warm-up with its compiles or
cache loads.  Its parts, which leave nothing between them, are logged on
earlier lines and read one by one by ``layer_metrics/setup_part_s.py``."""


def read(run, arg=None):
    return run.setup_s
