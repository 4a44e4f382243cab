"""Launch and transfer: the ``cop.transfer`` span, the host blocked in
``jax.device_get``: device execution plus D2H.  Median per class over the
sampled statements, geometric mean over classes."""

from harness.context import geomean_of_medians


def read(run, arg=None):
    return geomean_of_medians(run.span_ms("cop.transfer"))
