"""Device programs: Q3's share of its memory-bound roofline.  The bytes
its class file says the statement has to read (every column it reads of
``customer``, ``orders`` and ``lineitem``, once, at its narrow width;
nothing for the two lookups, the GROUP BY or the rank), over the device
kind's peak HBM bandwidth, over ``device_ms.q3`` (the device time of all
of the statement's launches).  Small by nature: a lookup costs its
indices, a gather an index of the probe side, not its bytes."""

from harness.roofline import scan_share


def read(run, arg=None):
    return scan_share(run, "q3")
