"""Device programs: ``device_ms.<class>``, the union of device-operation
intervals inside one statement of that class, from the profiler's trace;
median over the class's statements inside the traced slice.  Device time
is given to a class by which statement was in flight, so with more than
one client it is ambiguous and is not reported."""

from harness.context import median_or_none


def read(run, arg=None):
    if int(run.mix["clients"]) != 1:
        return None
    return median_or_none(run.device_ms().get(arg, []))
