"""Device, several chips: the part of ``collective_ms`` during which no
other operation ran on that device."""

from harness import xplane
from harness.context import median_or_none


def read(run, arg=None):
    if run.cell["chips"] == 1 or run.trace is None:
        return None
    per_class = xplane.exposed_collective_ms(
        run.trace, run.traced_statements(), run.trace_lo_ns, run.trace_hi_ns)
    return median_or_none([v for vs in per_class.values() for v in vs])
