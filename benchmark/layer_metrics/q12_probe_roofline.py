"""Device programs: Q12's share of its memory-bound roofline.  The bytes
its class file says a lookup join has to read (the probe columns once at
their narrow widths, the build columns once, nothing for the gather),
over the device kind's peak HBM bandwidth, over ``device_ms.q12``."""

from harness.roofline import scan_share


def read(run, arg=None):
    return scan_share(run, "q12")
