"""Launch and transfer: the ``cop.host_merge`` span, the host merging
per-device partial results.  Median per class over the sampled
statements, geometric mean over classes."""

from harness.context import geomean_of_medians


def read(run, arg=None):
    return geomean_of_medians(run.span_ms("cop.host_merge"))
