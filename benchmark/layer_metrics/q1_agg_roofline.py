"""Device programs: Q1's share of its memory-bound roofline.  The bytes
its class file says it must read (the seven columns, once, at their
narrow widths), over the device kind's peak HBM bandwidth, over
``device_ms.q1``: the same reduction as ``q6_scan_roofline``.  The dense
grouped reduction as one (G, N) int64 broadcast a SUM read 1.75 %; the
distance left to 100 is vector-unit work a row."""

from harness.roofline import scan_share


def read(run, arg=None):
    return scan_share(run, "q1")
