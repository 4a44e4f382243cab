"""Device, several chips: time inside collective operations per statement
(union on the device that spent longest in them), median over the
statements inside the traced slice."""

from harness import xplane
from harness.context import median_or_none


def read(run, arg=None):
    if run.cell["chips"] == 1:
        return None
    return median_or_none(
        [v for vs in run.device_ms(only=xplane.COLLECTIVE).values()
         for v in vs])
