"""Device programs: ``q3_x4``'s share of its memory-bound roofline on four
chips.  The bytes its class file says the statement has to read (every
column it reads of ``customer``, ``orders`` and ``lineitem``, once, at its
narrow width, spread over the cell's chips; nothing for the lookups, the
exchange, the GROUP BY or the rank), over the device kind's peak HBM
bandwidth, over ``device_ms.q3_x4`` (the device time of all of the
statement's launches on the chip that was busy longest).  Every column
has to be read once whatever the plan, so no plan passes 100."""

from harness.roofline import scan_share


def read(run, arg=None):
    return scan_share(run, "q3_x4")
