"""Launch and transfer: from the end of a program's execution on the
device (the last device to finish) to the end of the ``cop.device_wait``
span in which the statement thread was blocked on it: how long the host
takes to see that the device is done.  Median over the launches inside the
traced slice, ms.  One end is on the device's clock and the other on the
host's, which differ by up to a millisecond from trace to trace: see
``launch_latency_ms``, and ``launch_to_ready_ms`` for their sum."""

from harness import hostspans
from harness.context import median_or_none


def read(run, arg=None):
    if run.trace is None:
        return None
    return median_or_none([
        (x["ready"] - x["end"]) / 1e6 for x in hostspans.launches(
            run.trace, hostspans.of(run), run.trace_lo_ns, run.trace_hi_ns)
        if x["ready"] is not None])
