"""Device programs: ``q12_x4``'s share of its memory-bound roofline on
four chips: the bytes its class file says the statement has to read
(``lineitem``'s five columns and ``orders``' two, once, at their narrow
widths, spread over the cell's chips; nothing for the exchange or the
lookup) over the device kind's peak HBM bandwidth, over
``device_ms.q12_x4``.  Every column has to be read once whatever the
plan, so no plan passes 100."""

from harness.roofline import scan_share


def read(run, arg=None):
    return scan_share(run, "q12_x4")
