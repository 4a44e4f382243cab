"""Device programs: ``hndv_qty``'s share of its memory-bound roofline.
The bytes its class file says a grouped reduction has to read (the key and
the argument once, at their narrow widths), over the device kind's peak
HBM bandwidth, over ``device_ms.hndv_qty``.  Small by nature: the rows are
ordered by a sort, whose passes the bytes do not count."""

from harness.roofline import scan_share


def read(run, arg=None):
    return scan_share(run, "hndv_qty")
