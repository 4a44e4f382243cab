"""Launch and transfer: from the start of a ``sched.launch`` annotation
(the drain thread resolving and dispatching a program) to the start of
that program's execution on the device, paired by the program's name
(``harness/hostspans.launches``); median over the launches inside the
traced slice, ms.  One end is on the host's clock and the other on the
device's, and the two clocks of a trace differ by up to a millisecond,
another amount in every trace: the reading can be negative, and only its
sum with ``ready_latency_ms`` (``launch_to_ready_ms``) is free of that."""

from harness import hostspans
from harness.context import median_or_none


def read(run, arg=None):
    if run.trace is None:
        return None
    return median_or_none([
        (x["start"] - x["launch"]) / 1e6 for x in hostspans.launches(
            run.trace, hostspans.of(run), run.trace_lo_ns, run.trace_hi_ns)])
