"""Device programs: Q14's share of its memory-bound roofline.  The bytes
its class file says a lookup join has to read (probe columns once at
their narrow widths, build columns once, nothing for the gather), over
the device kind's peak HBM bandwidth, over ``device_ms.q14``."""

from harness.roofline import scan_share


def read(run, arg=None):
    return scan_share(run, "q14")
