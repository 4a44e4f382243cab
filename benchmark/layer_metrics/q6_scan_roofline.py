"""Device programs: Q6's share of its memory-bound roofline.  The bytes
its class file says it must read, over the device kind's peak HBM
bandwidth, over ``device_ms.q6``.  Memory-bound: Q6 does a handful of
integer operations per 9 bytes read."""

from harness.roofline import scan_share


def read(run, arg=None):
    return scan_share(run, "q6")
