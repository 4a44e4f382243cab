"""Device programs: ``hndv_rev``'s share of its memory-bound roofline.
The bytes its class file says a filtered grouped reduction has to read
(four columns once, at their narrow widths), over the device kind's peak
HBM bandwidth, over ``device_ms.hndv_rev``."""

from harness.roofline import scan_share


def read(run, arg=None):
    return scan_share(run, "hndv_rev")
