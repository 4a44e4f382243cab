"""Process start to the first statement of the window: import, server,
generate, register, ANALYZE, H2D, oracle, warm-up with its compiles or
cache loads.  Its parts are logged on earlier lines."""


def read(run, arg=None):
    return run.setup_s
