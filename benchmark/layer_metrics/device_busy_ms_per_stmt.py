"""Device: ``device_busy_ms_per_stmt``, the busy time of the traced
slice (the union of device-operation intervals, the mean over the
devices: ``xplane.busy``) over the statements answered wholly inside the slice,
in ms: what a statement costs the chip, whoever it shared a launch
with.  With one client it is the mean ``device_ms`` over the mix; with
several it is the only device time a statement can be given, and it
falls where statements share a scan (one execution for identical
statements in flight, one fused program for several).  Nothing to read
without a trace."""


def read(run, arg=None):
    busy = run.busy()
    if not busy:
        return None
    inside = sum(1 for _c, lo, hi in run.traced_statements()
                 if lo >= run.trace_lo_ns and hi <= run.trace_hi_ns)
    return busy["busy_s"] * 1e3 / inside if inside else None
