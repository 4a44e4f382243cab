"""Device programs: Q19's share of its memory-bound roofline, as
``q14_probe_roofline`` is Q14's."""

from harness.roofline import scan_share


def read(run, arg=None):
    return scan_share(run, "q19")
