"""Launch and transfer: what the host adds around one execution of a
device program: from the start of its ``sched.launch`` annotation to the
end of the ``cop.device_wait`` in which the statement thread saw it done
(both on the host's clock), less the program's own device time (a
duration, on any clock).  It is ``launch_latency_ms`` + ``ready_latency_ms``
taken launch by launch, without the offset between the host's and the
device's clock that each of those carries.  Median over the launches
inside the traced slice, ms."""

from harness import hostspans
from harness.context import median_or_none


def read(run, arg=None):
    if run.trace is None:
        return None
    return median_or_none([
        (x["ready"] - x["launch"] - (x["end"] - x["start"])) / 1e6
        for x in hostspans.launches(run.trace, hostspans.of(run),
                                    run.trace_lo_ns, run.trace_hi_ns)
        if x["ready"] is not None])
