"""Device programs: TopN's share of its memory-bound roofline.  The bytes
its class file says it must read (both ORDER BY columns, once), over the
device kind's peak HBM bandwidth, over ``device_ms.topn``: the same
reduction as ``q6_scan_roofline``.  A full sort of every row read 0.16 %;
the distance left to 100 says when TopN is finished."""

from harness.roofline import scan_share


def read(run, arg=None):
    return scan_share(run, "topn")
